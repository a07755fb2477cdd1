"""The comparison that decides ``correct``.

After the window, ``JOBS`` of the jobs the window completed are drawn
from the seed, and every lane of each (every node under every scheme)
is replayed by the plain reference
(:mod:`chipbench.reference`) on the job's own trace, regenerated from
``(seed, job)``, and compared with what the timed ``FleetProgram.run``
returned for that lane.  Three numbers, each the worst over those
lanes, are held against the limits in the configuration's file:

``exact_bytes_off``
    Largest byte difference in ``total_bytes`` and ``per_app_bytes``,
    which the configuration states exact (tolerance ``[0, 0]``): every
    byte lands once, on the node the sharding names.
``tol_used``
    Largest share of its stated tolerance that any other result field
    uses: ``|program - reference| / max(rtol * |reference|, atol)``.
    The stated tolerances are the device replay's documented
    approximations (stream-granular fills, flush anchors), so the limit
    is 1.
``hdd_clock_rel``
    Largest relative difference of ``io_seconds`` and ``total_seconds``
    on the ``orangefs`` lanes.  There every stream goes to the HDD, and
    the device replay adds the same per-stream times in the same order
    as the reference, so the two agree to double-precision rounding;
    this is the number that a replay computed in single precision fails.
"""

from __future__ import annotations

import numpy as np

from . import reference

JOBS = 2

RESULT_FIELDS = (
    "total_bytes", "bytes_to_ssd", "bytes_to_hdd_direct", "flushes",
    "peak_ssd_occupancy", "blocked_seconds", "flush_paused_seconds",
    "io_seconds", "total_seconds",
)
EXACT_FIELDS = ("total_bytes", "per_app_bytes")
CLOCK_FIELDS = ("io_seconds", "total_seconds")


def program_lane(sim) -> dict:
    """The compared fields of one lane of the program's result."""

    out = {f: getattr(sim, f) for f in RESULT_FIELDS}
    out["per_app_bytes"] = {str(k): int(v) for k, v in sim.per_app_bytes.items()}
    return out


def draw(seed: int, jobs: list[int], nodes: int) -> list[tuple[int, int]]:
    """``(job, node)`` pairs to check: every node of ``JOBS`` jobs drawn
    from the seed."""

    rng = np.random.default_rng([int(seed) % 2**64, 0xC4EC])
    picked = rng.choice(jobs, size=min(JOBS, len(jobs)), replace=False)
    return [(int(j), n) for j in sorted(picked) for n in range(nodes)]


def reference_lanes(cell, seed: int, picks, F=float) -> dict:
    """Reference results ``{(job, node, scheme): fields}`` of the picks,
    each job replayed on its own trace."""

    out = {}
    for job in sorted({j for j, _ in picks}):
        trace = cell.trace(seed, job)
        lane_cfg = cell.lane_config()
        for j, node in picks:
            if j != job:
                continue
            shard = reference.node_shard(trace, node, int(cell.cfg["nodes"]))
            for scheme in cell.cfg["schemes"]:
                out[(job, node, scheme)] = reference.replay_lane(
                    scheme, shard, lane_cfg, F)
    return out


def _shares(got: dict, want: dict, tolerances: dict):
    """``(key, field, share)`` for every toleranced field of every lane:
    ``|program - reference| / max(rtol * |reference|, atol)``."""

    for key, w in want.items():
        g = got[key]
        for f in RESULT_FIELDS:
            if f in EXACT_FIELDS:
                continue
            rtol, atol = tolerances[f]
            allowed = max(rtol * abs(w[f]), atol)
            diff = abs(float(g[f]) - float(w[f]))
            yield key, f, diff / allowed if allowed else (np.inf if diff else 0.0)


def numbers(got: dict, want: dict, tolerances: dict) -> dict[str, float]:
    """The compared numbers over lanes ``{key: fields}`` of the program
    (``got``) and the reference (``want``)."""

    exact = clock = 0.0
    for key, w in want.items():
        g = got[key]
        exact = max(exact, float(abs(g["total_bytes"] - w["total_bytes"])))
        apps = set(g["per_app_bytes"]) | set(w["per_app_bytes"])
        for a in apps:
            exact = max(exact, float(abs(g["per_app_bytes"].get(a, 0)
                                         - w["per_app_bytes"].get(a, 0))))
        if key[2] == "orangefs":
            for f in CLOCK_FIELDS:
                clock = max(clock, abs(float(g[f]) - float(w[f])) / abs(float(w[f])))
    tol = max((share for _, _, share in _shares(got, want, tolerances)), default=0.0)
    return {"exact_bytes_off": exact, "tol_used": tol, "hdd_clock_rel": clock}


def offenders(got: dict, want: dict, tolerances: dict, k: int = 8) -> list[str]:
    """The ``k`` lane fields that use most of their stated tolerance, as
    ``job/node/scheme field: program vs reference (share)`` lines."""

    rows = sorted(((share, key, f) for key, f, share in _shares(got, want, tolerances)
                   if share > 1), key=lambda r: -r[0])
    if not rows:
        return []
    return [f"{len(rows)} lane fields over tolerance"] + [
        f"{key[0]}/{key[1]}/{key[2]} {f}: {got[key][f]!r} vs {want[key][f]!r} "
        f"({share:.4g} of its tolerance)" for share, key, f in rows[:k]]


def verdict(values: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: each number must not
    exceed its limit."""

    table = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"] for v in table.values())
    return bool(ok), table

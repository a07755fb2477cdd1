"""Host milliseconds per job in ``FleetProgram.run`` outside the layer
calls it makes (sharding, scoring, tape build, replay): lane structs and
result assembly."""

CHILDREN = ("shard", "score", "build_events", "stack_events", "replay_lanes")


def read(run):
    if not run.jobs:
        return None
    own = run.spans.total("fleet_run") - run.spans.total(*CHILDREN)
    return own / run.jobs * 1e3

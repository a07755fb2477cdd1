"""Host milliseconds per job building event tapes: ``build_events`` for
every shard and ``stack_events`` for the lanes."""


def read(run):
    if not run.jobs:
        return None
    return run.spans.total("build_events", "stack_events") / run.jobs * 1e3

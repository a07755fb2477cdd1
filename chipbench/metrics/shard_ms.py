"""Host milliseconds per job in ``FleetProgram.shard`` (``assign_nodes``
and ``TraceBatch.shard``)."""


def read(run):
    return run.spans.total("shard") / run.jobs * 1e3 if run.jobs else None

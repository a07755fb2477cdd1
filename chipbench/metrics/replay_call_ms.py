"""Host milliseconds per job around ``replay_lanes``: upload, the replay
program on the device, and readback."""


def read(run):
    return run.spans.total("replay_lanes") / run.jobs * 1e3 if run.jobs else None

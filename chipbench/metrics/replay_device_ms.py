"""Device milliseconds per job of the replay program (``_replay_program``,
``scan(vmap(_event_step))`` plus the final drain), from the trace."""

from chipbench.profile import program_seconds


def read(run):
    if run.trace is None or not run.jobs:
        return None
    return program_seconds(run.trace, "_replay_program") / run.jobs * 1e3

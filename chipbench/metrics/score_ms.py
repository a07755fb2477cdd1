"""Host milliseconds per job in all ``compute_stream_scores`` calls:
stream-matrix padding, dispatch, device run and readback."""


def read(run):
    return run.spans.total("score") / run.jobs * 1e3 if run.jobs else None

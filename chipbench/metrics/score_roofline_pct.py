"""Share of its roofline that the scoring program (``_stream_stats64``)
reaches: the least time its bytes need at the chip's HBM bandwidth, over
its device time in the trace.  The work is memory-bound (a sort of 128
int64 keys per stream), so bytes set the bound: each request's offset
and size read once, each stream's three results written once, counted
from shapes by the span wrapper (``chipbench.spans``)."""

from chipbench.profile import program_seconds


def read(run):
    if run.trace is None:
        return None
    secs = program_seconds(run.trace, "_stream_stats64")
    least = run.spans.counters["score_bytes"] / run.peak("hbm_bytes_per_s")
    return 100.0 * least / secs

"""Trace requests x schemes of every job the window completed, over the
time from the window's start to the last completion (host clock)."""


def read(run):
    return run.work / run.window_s if run.jobs else None

"""Tail ops caught up per second: the ops of every document answered at
its head in the window, over the whole window (first request sent to
last answer received)."""


def read(run):
    return run["ops_folded"] / run["window_s"]

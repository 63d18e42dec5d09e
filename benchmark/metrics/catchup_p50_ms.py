"""Median catch-up latency over all the window's requests, each from when
it was due to when its final answer arrived (held sheds and resends
included; a request that never got an answer counts at its give-up
time)."""

from benchmark.stats import nearest_rank


def read(run):
    return 1000.0 * nearest_rank(run["latencies_s"], 50)

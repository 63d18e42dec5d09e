"""95th-percentile catch-up latency over all the window's requests, each
from when it was due to when its final answer arrived (held sheds and
resends included; a request that never got an answer counts at its
give-up time).  Read in the traced run: about one run in seven meets a
shed storm that lifts it tenfold or more, so it carries no bound."""

from benchmark.stats import nearest_rank


def read(run):
    return 1000.0 * nearest_rank(run["latencies_s"], 95)

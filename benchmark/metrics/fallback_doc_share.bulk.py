"""Share of the documents answered at their head whose fold left the
device: whole documents folded by the CPU container path (``cpuDocs``)
plus kernel channels that fell back to their host fold
(``fallbackChannels``; one channel per document here), summed over the
window's answers."""


def read(run):
    if not run["docs_fresh"]:
        return None
    off = run["answers"]["cpuDocs"] + run["answers"]["fallbackChannels"]
    return 100.0 * off / run["docs_fresh"]

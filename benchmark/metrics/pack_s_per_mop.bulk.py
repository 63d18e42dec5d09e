"""Busy seconds of the host pack stage (``pipeline_stage["pack"]``, summed
over its worker threads, over the window) per million ops folded."""


def read(run):
    if not run["ops_folded"] or "pack" not in run["stage"]:
        return None
    return run["stage"]["pack"] / (run["ops_folded"] / 1e6)

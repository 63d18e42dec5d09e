"""Seconds of host oracle folds of SharedString and SharedTree documents
routed off the device (``pipeline_stage["fallback"]``: before the pack
and after the fold, summed over the extract threads), over the window,
per million ops folded."""


def read(run):
    if not run["ops_folded"] or "fallback" not in run["stage"]:
        return None
    return run["stage"]["fallback"] / (run["ops_folded"] / 1e6)

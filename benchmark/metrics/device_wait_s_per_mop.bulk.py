"""Seconds the family pipeline's device thread spent waiting on the chip
(``pipeline_stage["device_wait"]``, over the window) per million ops
folded."""


def read(run):
    if not run["ops_folded"] or "device_wait" not in run["stage"]:
        return None
    return run["stage"]["device_wait"] / (run["ops_folded"] / 1e6)

"""Seconds of the catch-up service's serial host work around the family
pipeline (``pipeline_stage`` keys ``prepare``, ``assemble`` and
``publish``: reading and decoding tails, building kernel inputs,
assembling container summaries, publishing and uploading them), over the
window, per million ops folded."""

KEYS = ("prepare", "assemble", "publish")


def read(run):
    if not run["ops_folded"] or any(k not in run["stage"] for k in KEYS):
        return None
    return sum(run["stage"][k] for k in KEYS) / (run["ops_folded"] / 1e6)

"""Share of the traced window in which no op ran on the chip."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

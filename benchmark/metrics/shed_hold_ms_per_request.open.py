"""Milliseconds of ``retryAfter`` the server handed out on sheds
(``catchup.retry_after_s``: the time shed callers hold before resending)
over the window, per request the window sent."""


def read(run):
    if "catchup.retry_after_s" not in run["server"] or not run["requests"]:
        return None
    return run["server"]["catchup.retry_after_s"] * 1000 / run["requests"]

"""Overload nacks per request: the server's ``catchup.shed`` counter over
the window, per request the window sent (each shed costs its request a
held wait and a resend)."""


def read(run):
    if not run["requests"]:
        return None
    return run["server"].get("catchup.shed", 0) / run["requests"]

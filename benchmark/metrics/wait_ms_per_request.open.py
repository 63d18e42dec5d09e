"""Milliseconds a fold-lane request waited inside the server before its
work ran: for an executor thread (server ``catchup.queued_s``) and for
the catch-up service's fold lock (``pipeline_stage["serial_wait"]``),
over the window, per fold-lane request the server counted
(``catchup.requests``, resends after a shed included)."""


def read(run):
    server, stage = run["server"], run["stage"]
    if "catchup.queued_s" not in server or "serial_wait" not in stage \
            or not server.get("catchup.requests"):
        return None
    return (server["catchup.queued_s"] + stage["serial_wait"]) * 1000 \
        / server["catchup.requests"]

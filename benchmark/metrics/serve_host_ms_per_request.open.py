"""Milliseconds of host work per admitted fold: the server's
``catchup.serve_s`` (admitted folds, from lease to answer) less the
fold-lock wait (``pipeline_stage["serial_wait"]``) and the wait on the
chip (``pipeline_stage["device_wait"]``), over the window, per
``catchup.admitted``."""


def read(run):
    server, stage = run["server"], run["stage"]
    if "catchup.serve_s" not in server or "serial_wait" not in stage \
            or "device_wait" not in stage \
            or not server.get("catchup.admitted"):
        return None
    host = server["catchup.serve_s"] - stage["serial_wait"] \
        - stage["device_wait"]
    return host * 1000 / server["catchup.admitted"]

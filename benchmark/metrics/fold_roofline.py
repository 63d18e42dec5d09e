"""The fold's share of its HBM roofline in the traced window.

The least time the chip could take is the least bytes the work must move
over the chip's HBM rate (``peaks.json``).  The bytes count the work
only, never the program's layout: each op folded is read once as a
record of OP_RECORD_BYTES (kind, seq, client, ref seq, min seq and three
operands, as int32), and each summary answered is written once, counted
as the bytes of its channel blobs as the client receives them.  The
share is that least time over the chip's busy time in the window, which
covers every op the device ran (fold, export and any other)."""

OP_RECORD_BYTES = 32


def least_bytes(ops_folded: int, summary_bytes: int) -> int:
    return ops_folded * OP_RECORD_BYTES + summary_bytes


def read(run):
    trace = run["trace"]
    if trace is None or not trace["busy_s"] or not run["ops_folded"]:
        return None
    least_s = least_bytes(run["ops_folded"], run["summary_bytes"]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]

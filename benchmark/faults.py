"""Faults planted under the timed path, to show that ``correct`` catches
them.  Each is a context manager that patches the program in this
process only; the benchmark's own runs plant none.

- ``stale_tail`` — the control: catch-up folds each tail but its last
  op, breaking the configuration's guarantee that an answer holds every
  op acknowledged before it (the freshness a reader loads with);
- ``unchanged_state`` — the fold hands back each document's state
  unchanged (the base summary) under the new seq;
- ``half_batch`` — the answer leaves out half of the documents asked;
- ``altered_answer`` — one document of every folded batch is changed
  where the fold produces it.
"""

from __future__ import annotations

import contextlib
import json


@contextlib.contextmanager
def _patched(owner, name: str, make):
    original = getattr(owner, name)
    setattr(owner, name, make(original))
    try:
        yield
    finally:
        setattr(owner, name, original)


def _stale_tail():
    from fluidframework_tpu.service.oplog import OpLog

    def make(get):
        def get_all_but_last(self, doc_id, *args, **kwargs):
            tail = get(self, doc_id, *args, **kwargs)
            return tail[:-1] if len(tail) > 1 else tail
        return get_all_but_last
    return _patched(OpLog, "get", make)


def _unchanged_state():
    from fluidframework_tpu.service.catchup import CatchupService

    def make(device_fold):
        def base_summaries(self, works):
            device_fold(self, works)
            return [work.summary for work in works]
        return base_summaries
    return _patched(CatchupService, "_device_fold", make)


def _half_batch():
    from fluidframework_tpu.service.server import OrderingServer

    def make(respond):
        def half(self, client, catchup, prefix, doc_ids, results, *a, **k):
            keep = dict(sorted(results.items())[:len(results) // 2])
            return respond(self, client, catchup, prefix, doc_ids, keep,
                           *a, **k)
        return half
    return _patched(OrderingServer, "_catchup_response", make)


def _alter(tree) -> None:
    """Change one value inside the first channel of a container summary."""
    from fluidframework_tpu.protocol.summary import SummaryBlob

    datastores = tree.children[".datastores"]
    ds = next(iter(datastores.children.values()))
    channel = next(v for k, v in sorted(ds.children.items())
                   if not k.startswith("."))
    if "body" in channel.children:
        records = json.loads(channel.children["body"].content)
        for rec in records:
            if rec["t"]:
                rec["t"] = ("b" if rec["t"][0] == "a" else "a") + rec["t"][1:]
                break
        channel.children["body"] = SummaryBlob(
            json.dumps(records, sort_keys=True,
                       separators=(",", ":")).encode())
    else:
        header = json.loads(channel.children["header"].content)
        header["seq"] = header.get("seq", 0) + 1
        channel.children["header"] = SummaryBlob(
            json.dumps(header, sort_keys=True,
                       separators=(",", ":")).encode())


def _altered_answer():
    from fluidframework_tpu.service.catchup import CatchupService

    def make(device_fold):
        def altered(self, works):
            trees = device_fold(self, works)
            if trees:
                _alter(trees[0])
            return trees
        return altered
    return _patched(CatchupService, "_device_fold", make)


FAULTS = {
    "stale_tail": _stale_tail,
    "unchanged_state": _unchanged_state,
    "half_batch": _half_batch,
    "altered_answer": _altered_answer,
}


def planted(name):
    """The context manager that plants fault ``name`` (None: nothing)."""
    if name is None:
        return contextlib.nullcontext()
    return FAULTS[name]()

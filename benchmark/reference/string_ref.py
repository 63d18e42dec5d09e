"""Plain SharedString reference: the document state after a sequenced tail.

Written from the merge-tree rules (upstream merge-tree, and the
repository's SEMANTICS.md), not from the program's code, and importing
nothing of it.  Ops apply in seq order; each resolves its positions in
its author's view ``(ref_seq, client)``: a character is in that view
when its insert is at or below ``ref_seq`` or by that client, and its
removal (if any) is neither at or below ``ref_seq`` nor by that client,
as remover or overlapping remover.

- insert at ``pos``: the new characters go right after the ``pos``-th
  character of the view (at the start for 0), before anything that
  follows it: concurrent inserts the view lacks and removed characters
  (a walk stops before the first sequenced segment of any kind), so
  concurrent inserts at one place stack newest first;
- remove ``[start, end)``: the characters of the view there take the
  remover's ``(seq, client)``; one already removed by an op the view
  lacks keeps the first remover and records this client as an
  overlapping remover; removed characters stay in place;
- annotate ``[start, end)``: each key of ``props`` is set on the
  characters of the view there (``None`` deletes the key): the last op
  in seq order wins.

The state is one entry per character, in order, removed ones included:
``(char, insert seq, insert client, removed seq, removed client,
overlapping removers, props)``, with the removers and the props as sorted
tuples.  The window floor is the highest ``min_seq`` of the ops applied:
characters removed at or below it are collected, and inserts at or below
it read seq 0 and client ``None``, as a summary writes them.
"""

from __future__ import annotations

import json

# one character: [char, seq, client, removed seq, removed client,
#                 overlapping removers (set), props (dict)]
S, C, RS, RC, RO, P = 1, 2, 3, 4, 5, 6


def _in_view(ch: list, ref: int, client: str) -> bool:
    if ch[S] > ref and ch[C] != client:
        return False
    rs = ch[RS]
    return rs is None or (rs > ref and ch[RC] != client
                          and client not in ch[RO])


def _view_index(chars: list, pos: int, ref: int, client: str) -> int:
    """Index just after the ``pos``-th character of the view (0 for 0)."""
    if pos == 0:
        return 0
    seen = 0
    for i, ch in enumerate(chars):
        if _in_view(ch, ref, client):
            seen += 1
            if seen == pos:
                return i + 1
    raise ValueError(f"insert at {pos} beyond the {seen} characters of "
                     f"the view")


def _view_range(chars: list, start: int, end: int, ref: int,
                client: str) -> list:
    out, seen = [], 0
    for ch in chars:
        if _in_view(ch, ref, client):
            if start <= seen < end:
                out.append(ch)
            seen += 1
            if seen >= end:
                break
    if seen < end:
        raise ValueError(f"range [{start}, {end}) beyond the {seen} "
                         f"characters of the view")
    return out


def string_state(ops: list, upto_seq: int) -> tuple:
    """``(header, per-character state)`` after the ops with seq <=
    ``upto_seq``; the header is ``{"length", "minSeq", "seq"}``."""
    chars: list = []
    seq_now, min_seq = 0, 0
    for seq, client, ref, op_min_seq, op in ops:
        if seq > upto_seq:
            break
        seq_now, min_seq = seq, max(min_seq, op_min_seq)
        kind = op["kind"]
        if kind == "insert":
            i = _view_index(chars, op["pos"], ref, client)
            chars[i:i] = [[c, seq, client, None, None, set(), {}]
                          for c in op["text"]]
        elif kind == "remove":
            for ch in _view_range(chars, op["start"], op["end"], ref,
                                  client):
                if ch[RS] is None:
                    ch[RS], ch[RC] = seq, client
                else:
                    ch[RO].add(client)
        elif kind == "annotate":
            for ch in _view_range(chars, op["start"], op["end"], ref,
                                  client):
                for key, value in op["props"].items():
                    if value is None:
                        ch[P].pop(key, None)
                    else:
                        ch[P][key] = value
        else:
            raise ValueError(f"unknown op kind {kind!r}")
    state = []
    for c, s, cl, rs, rc, ro, props in chars:
        if rs is not None and rs <= min_seq and s <= min_seq:
            continue  # collected once the window floor passes it
        if s <= min_seq:
            s, cl = 0, None
        state.append((c, s, cl, rs, rc, tuple(sorted(ro)),
                      tuple(sorted(props.items()))))
    header = {"length": sum(1 for ch in chars if ch[RS] is None),
              "minSeq": min_seq, "seq": seq_now}
    return header, state


# -- the comparison: a served summary against this reference ---------------
#
# The served summary is read as a client reads it: the channel's blobs
# (``header`` and ``body``), as the canonical JSON they are stored in.


def _string_chars(records: list) -> list:
    out = []
    for rec in records:
        extra = {k: rec[k] for k in rec
                 if k not in ("t", "s", "c", "rs", "rc", "ro", "p")}
        if extra:
            raise ValueError(f"record keys the reference never makes: "
                             f"{sorted(extra)}")
        props = tuple(sorted(rec.get("p", {}).items()))
        removers = tuple(sorted(rec.get("ro", ())))
        for ch in rec["t"]:
            out.append((ch, rec["s"], rec["c"], rec.get("rs"),
                        rec.get("rc"), removers, props))
    return out


def check(blobs: dict, ops: list, seq: int):
    if set(blobs) != {"header", "body"}:
        return f"channel blobs {sorted(blobs)}"
    header, state = string_state(ops, seq)
    got_header = json.loads(blobs["header"])
    if got_header != header:
        return f"header {got_header} != reference {header}"
    try:
        got = _string_chars(json.loads(blobs["body"]))
    except ValueError as exc:
        return str(exc)
    if got != state:
        first = next((i for i, (a, b) in enumerate(zip(got, state))
                      if a != b), min(len(got), len(state)))
        return (f"state differs at character {first} of {len(state)} "
                f"(served {len(got)})")
    return None
